"""The NAM store: the shared distributed memory pool (paper §2.1, §5).

A :class:`NAMStore` bundles the unified versioned record pool (one
:class:`~repro.core.mvcc.VersionedTable` whose slot space is carved into
tables by the :class:`~repro.core.catalog.Catalog`), the timestamp-vector
oracle state, and the extend-based allocator for inserts (§5.3).

Distribution: :func:`distributed_round` executes one SI round with the pool
**range-partitioned over a mesh axis** via ``shard_map`` — each device is one
memory server. One-sided reads become masked local gathers + an
``all-reduce`` combine; CAS/installs are arbitrated and applied only by the
owning shard; the commit decision is a global AND (``psum`` of per-shard
failure counts). This is the JAX-native rendering of the paper's one-sided
access pattern (see DESIGN.md §2) — no shard ever runs another shard's
transaction logic hand-shake, mirroring "memory servers are dumb".
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import annotations as anno
from repro.core import cas, gc as gc_ops, hashtable as ht, header as hdr_ops, \
    mvcc, wal
from repro.core.catalog import Catalog
from repro.core.mvcc import VersionedTable
from repro.core.si import TxnBatch
from repro.core.tsoracle import VectorOracle, VectorState


class ExtendState(NamedTuple):
    """§5.3 extend allocator: each (thread, table-region) owns a contiguous
    extend of slots; inserts bump a private cursor — no allocation RPC in the
    critical path and no cross-thread contention, as in the paper."""
    cursor: jnp.ndarray  # int32 [n_threads, n_regions]


class NAMStore(NamedTuple):
    table: VersionedTable
    oracle_state: VectorState
    extends: ExtendState


def init_store(catalog: Catalog, oracle: VectorOracle, *, n_old: int = 2,
               n_overflow: int = 2, width: int | None = None,
               n_insert_regions: int = 1) -> NAMStore:
    """Build the NAM store for a catalog: versioned pool + oracle + extends.

    Every record starts *existing* (found by reads). Insert-style regions
    must start non-existent so reads report not-found until an extend install
    creates the record — the catalog carries no layout knowledge of strided
    extends, so that is the **caller's obligation**: after ``init_store``,
    pre-mark each insert region via :func:`mark_region_deleted` (contiguous
    regions) or :func:`mark_slots_deleted` (strided layouts, e.g. the
    warehouse-major TPC-C pool).
    """
    w = width or max(s.width for s in catalog.specs.values())
    tbl = mvcc.init_table(catalog.total_records, w, n_old=n_old,
                          n_overflow=n_overflow)
    return NAMStore(
        table=tbl,
        oracle_state=oracle.init(),
        extends=ExtendState(
            cursor=jnp.zeros((oracle.n_threads, n_insert_regions), jnp.int32)),
    )


def mark_region_deleted(store: NAMStore, base: int, count: int) -> NAMStore:
    """Pre-mark an insert region's records as deleted (non-existent)."""
    return mark_slots_deleted(store, jnp.arange(base, base + count))


def mark_slots_deleted(store: NAMStore, slots) -> NAMStore:
    """Pre-mark arbitrary record slots as deleted (non-existent) — used for
    strided insert regions (e.g. the warehouse-major TPC-C layout)."""
    slots = jnp.asarray(slots, jnp.int32)
    meta = store.table.cur_hdr[:, hdr_ops.META]
    meta = meta.at[slots].set(meta[slots] | hdr_ops.DELETED_BIT)
    return store._replace(
        table=store.table._replace(
            cur_hdr=store.table.cur_hdr.at[:, hdr_ops.META].set(meta)))


def allocate(extends: ExtendState, tid, region, n, region_base, extend_size,
             threads: int):
    """Allocate ``n`` slots from thread ``tid``'s extend of ``region``.

    Returns (new_extends, first_slot). Layout: region records are striped as
    ``region_base + tid*extend_size + cursor`` — the compute server computed
    the remote address itself, no RPC (one-sided allocation).
    """
    cur = extends.cursor[tid, region]
    first = region_base + tid * extend_size + cur
    new = extends.cursor.at[tid, region].add(n)
    return ExtendState(cursor=new), first


# ---------------------------------------------------------------------------
# §5.2 hash index: the store-level directory over the record pool
# ---------------------------------------------------------------------------
def build_directory(keys, slots, n_buckets: int, *,
                    max_probes: int = 16) -> ht.HashTable:
    """Bulk-build the key → record-slot hash index (paper §5.2).

    Uses the same ``max_probes`` the lookups will use, so every entry that
    places is guaranteed findable. Probe exhaustion
    (``hashtable.insert``'s ``placed_at == -1``) is a *load* error, not a
    condition a caller may silently drop — an unplaced key would make every
    later lookup of it report not-found and the engine would treat a loaded
    record as nonexistent. Raise instead; callers size ``n_buckets`` up.
    """
    keys = jnp.asarray(keys, jnp.uint32)
    slots = jnp.asarray(slots, jnp.int32)
    table = ht.init(n_buckets)
    table, placed = ht.insert(table, keys, slots, max_probes=max_probes)
    n_dropped = int(jnp.sum(placed < 0))
    if n_dropped:
        raise ValueError(
            f"directory build dropped {n_dropped}/{keys.shape[0]} keys: "
            f"probe chains exceeded max_probes={max_probes} at "
            f"{n_buckets} buckets (load factor "
            f"{keys.shape[0] / n_buckets:.2f}) — grow the bucket array")
    return table


def shard_directory(mesh: Mesh, axis: str, directory: ht.HashTable):
    """Range-partition the bucket array over the memory-server mesh axis —
    the §5.2 placement (``hashtable.partition_of`` names the owner of a
    key's home bucket under this split). The bucket count must divide
    evenly, as with :func:`pad_table` for records."""
    n_shards = mesh.shape[axis]
    if directory.n_buckets % n_shards:
        raise ValueError(f"directory has {directory.n_buckets} buckets, not "
                         f"divisible over {n_shards} memory servers")
    def put(x):
        return jax.device_put(x, NamedSharding(mesh, P(axis)))
    return ht.HashTable(keys=put(directory.keys), vals=put(directory.vals))


# ---------------------------------------------------------------------------
# Distributed execution: one SI round under shard_map
# ---------------------------------------------------------------------------
class DistRoundOut(NamedTuple):
    """Replicated per-round outputs of :func:`distributed_round`.

    Mirrors :class:`repro.core.si.RoundResult` minus the state (table and
    timestamp vector travel separately because they stay device-sharded);
    the trailing counters feed :func:`repro.core.si.count_ops` so the
    distributed path produces the same RDMA-op accounting as the
    single-shard reference.
    """
    committed: jnp.ndarray      # bool  [T]
    snapshot_miss: jnp.ndarray  # bool  [T]
    read_data: jnp.ndarray      # int32 [T, RS, W]
    txn_found: jnp.ndarray      # bool  [T]
    from_current: jnp.ndarray   # bool  [T, RS] — read hit the in-place version
    from_ovf: jnp.ndarray       # bool  [T, RS] — served by the overflow region
    read_found: jnp.ndarray     # bool  [T, RS] — raw per-read visibility
    n_installs: jnp.ndarray     # int32 [] — installs across all shards
    n_releases: jnp.ndarray     # int32 [] — abort-path lock releases


def _local_slots(slots, base, count):
    """Map global slots to local; out-of-shard → count (OOB, dropped)."""
    loc = slots - base
    inside = (loc >= 0) & (loc < count)
    return jnp.where(inside, loc, count), inside


def distributed_round(mesh: Mesh, axis: str, oracle: VectorOracle,
                      compute_fn: Callable, shard_records: int, *,
                      shard_vector: bool = False, n_dir_buckets: int = 0,
                      dir_max_probes: int = 16, with_journal: bool = False,
                      fused_commit: bool = False,
                      batched_probe: bool = False):
    """Build a jittable ``round(table_sharded, vec, batch, aux)`` executor.

    ``table_sharded``: VersionedTable with leading record axis sharded over
    ``axis`` — each device is one memory server owning ``shard_records``
    contiguous pool slots. ``batch`` (and the ``aux`` pytree threaded to
    ``compute_fn``) is replicated: every memory server sees every request and
    applies only its own slots — the all-gather of requests is the
    message-pattern dual of one-sided reads and is counted as such by the
    cost model, not as two-sided RPC handling.

    ``compute_fn(read_hdr, read_data, vec, aux) -> new_data`` is the
    transaction logic; ``aux`` carries per-round inputs (e.g. the TPC-C
    order lines) so one built executor serves every round.

    ``shard_vector=True`` additionally range-partitions the timestamp vector
    over the same mesh axis (§4.2 "Partitioning of T_R", the
    :class:`~repro.core.tsoracle.PartitionedVectorOracle` deployment): each
    memory server owns ``n_slots / n_shards`` contiguous vector slots, the
    snapshot read becomes an all-gather of the parts, and each server writes
    back only its own part. Semantics are identical to the replicated vector
    — the partitioning is a placement decision, exactly as in the paper.

    ``n_dir_buckets > 0`` enables the §5.2 key-addressed read path: the hash
    index's bucket array is range-partitioned over the same axis (each
    memory server owns ``n_dir_buckets / n_shards`` contiguous buckets, see
    :func:`shard_directory`) and ``round_fn`` grows keyword arguments
    ``directory`` (the sharded :class:`~repro.core.hashtable.HashTable`),
    ``read_keys`` and ``key_mask`` (replicated ``[T, RS]``): marked reads
    resolve their record slot by probing the partitioned directory — every
    server walks the probe sequence over its resident buckets
    (:func:`~repro.core.hashtable.lookup_shard`) and an all-reduce
    reconstructs the lookup — then validate/install at the resolved slot,
    bit-identical to :func:`repro.core.si.run_round`'s key mode.

    ``with_journal=True`` wires the §6.2 WAL through the round: ``round_fn``
    grows keyword arguments ``journal`` (a :class:`~repro.core.wal.Journal`
    whose replica axis is mapped over the mesh axis — one resident replica
    per memory server, see :func:`shard_journal`), ``round_no`` and ``seq``;
    every server appends the round's intent records to its own replica
    *before* install and the outcome record after the global commit
    decision (identical per-server content — the broadcast journal write),
    and the updated journal is returned as a fourth output. A server
    failure therefore leaves surviving replicas to replay from.

    ``fused_commit`` / ``batched_probe`` swap per-shard protocol phases for
    the Pallas kernels (DESIGN.md §8) — access-path choices, never
    semantics, proven bit-identical through the equivalence harness
    (tests/_distributed_equiv_check.py with ``REPRO_EQUIV_FUSED=1``).
    ``batched_probe`` resolves each server's masked local read-set in one
    locate-only kernel launch (key resolution stays the partitioned
    ``lookup_shard`` + psum — the bucket array is range-partitioned).
    ``fused_commit`` replaces validate/lock/install/release/make-visible
    with the commit kernel's decide/apply double-launch: the decide pass
    contributes this shard's failure counts to the global-AND psum, the
    apply pass replays with ``ext_fails = total - local``.

    Returns ``(round_fn, n_shards)`` with
    ``round_fn(table, vec, batch, aux, active=None) -> (table, vec,
    DistRoundOut[, journal])``. ``active`` (bool [T], default all-true)
    marks the threads running a transaction this round — the mixed-workload
    sub-round mask of :func:`repro.core.si.run_round`: inactive threads
    issue no CAS and publish no commit timestamp.
    """
    n_shards = mesh.shape[axis]
    if shard_vector:
        # ceil-partition: a vector whose length does not divide the shard
        # count (a 3→5-style expansion) is zero-padded to the next multiple
        # (:func:`pad_vector`); the padding is stripped right after the
        # all-gather, so every slot of transaction logic sees the exact
        # unpadded vector — bit-identical to the replicated deployment
        part_slots = -(-oracle.n_slots // n_shards)
        padded_slots = part_slots * n_shards
    if n_dir_buckets and n_dir_buckets % n_shards:
        raise ValueError(f"n_dir_buckets ({n_dir_buckets}) must divide over "
                         f"the mesh axis ({n_shards})")

    def local_round(table: VersionedTable, vec: jnp.ndarray, batch: TxnBatch,
                    aux, active, *extra):
        if with_journal:
            journal, jround, jseq = extra[:3]
            dir_args = extra[3:]
        else:
            journal, dir_args = None, extra
        shard_id = jax.lax.axis_index(axis)
        base = shard_id * shard_records
        T, RS = batch.read_slots.shape
        WS = batch.write_ref.shape[1]
        W = table.payload_width

        with jax.named_scope("si.read"):
            # ---- 1. read the timestamp vector (gather the partitions) -------
            if shard_vector:
                vec = jax.lax.all_gather(vec, axis, tiled=True)
                if padded_slots != oracle.n_slots:
                    vec = vec[:oracle.n_slots]

            # ---- 2a. key resolution against the partitioned directory (§5.2)
            if n_dir_buckets:
                dir_keys, dir_vals, read_keys, key_mask = dir_args
                dir_base = shard_id * (n_dir_buckets // n_shards)
                vsum, khit = ht.lookup_shard(
                    dir_keys, dir_vals, read_keys.reshape(-1), dir_base,
                    n_dir_buckets, max_probes=dir_max_probes)
                vsum = jax.lax.psum(vsum, axis)
                khit = jax.lax.psum(khit.astype(jnp.int32), axis) > 0
                kfound = khit & (vsum >= 0)
                km = key_mask.reshape(-1)
                flat = jnp.where(km, jnp.where(kfound, vsum, 0),
                                 batch.read_slots.reshape(-1))
                key_ok = ~km | kfound
            else:
                flat = batch.read_slots.reshape(-1)
                key_ok = jnp.ones(flat.shape, bool)
            read_slots = flat.reshape(T, RS)     # resolved slots, used below

            # ---- 2b. one-sided visible reads (masked local + all-reduce) ----
            loc, inside = _local_slots(flat, base, shard_records)
            safe = jnp.where(inside, loc, 0)
            if batched_probe:
                # batched-probe kernel in locate-only mode: each memory server
                # resolves its masked local slots in ONE launch, then a single
                # payload gather (DESIGN.md §8). Key resolution stays the
                # partitioned lookup_shard + psum above — the bucket array is
                # range-partitioned, so no single shard can walk a whole probe
                # sequence. gather_version over the kernel's locator reproduces
                # read_visible bit-exactly (the lock-step-oracle contract), so
                # the psum/masking combine below is untouched.
                from repro.kernels.hash_probe import ops as probe_ops
                _, f_loc, src, pos = probe_ops.batched_probe(
                    None, None, table, vec, safe, None, None)
                hdr_f, data_f = mvcc.gather_version(
                    table, safe,
                    mvcc.VersionLoc(found=f_loc, src=src, pos=pos))
                vr = mvcc.VisibleRead(
                    hdr=hdr_f, data=data_f, found=f_loc,
                    from_current=f_loc & (src == mvcc.SRC_CURRENT),
                    from_ovf=f_loc & (src == mvcc.SRC_OVF))
            else:
                vr = mvcc.read_visible(table, safe, vec)
            rh = jnp.where(inside[:, None], vr.hdr, 0)
            rd = jnp.where(inside[:, None], vr.data, 0)
            fnd = jnp.where(inside, vr.found, False)
            fcur = jnp.where(inside, vr.from_current, False)
            fovf = jnp.where(inside, vr.from_ovf, False)
            rh = jax.lax.psum(rh, axis)
            rd = jax.lax.psum(rd, axis)
            # key_ok masks a directory miss's visibility outcomes wholesale
            # (the miss resolved to the safe slot 0) — identically to
            # si.run_round, so the two paths' telemetry cannot diverge
            read_found = ((jax.lax.psum(fnd.astype(jnp.int32), axis) > 0)
                          & key_ok).reshape(T, RS)
            from_current = ((jax.lax.psum(fcur.astype(jnp.int32), axis) > 0)
                            & key_ok).reshape(T, RS)
            from_ovf = ((jax.lax.psum(fovf.astype(jnp.int32), axis) > 0)
                        & key_ok).reshape(T, RS)
            read_hdr = rh.reshape(T, RS, 2).astype(jnp.uint32)
            read_data = rd.reshape(T, RS, W)
            found = read_found | ~batch.read_mask
            txn_found = jnp.all(found, axis=1)

        # ---- 3. local transaction logic (replicated, deterministic) ------
        new_data = compute_fn(read_hdr, read_data, vec, aux)

        # ---- 4. commit timestamps, created locally (same as si.run_round)
        slot_ids = oracle.slot_of_thread(batch.tid)
        if hasattr(oracle, "next_commit_ts_batch"):
            cts = oracle.next_commit_ts_batch(
                VectorState(vec=vec), batch.tid, txn_found & active)
        else:
            cts = vec[slot_ids] + jnp.uint32(1)
        new_hdr = hdr_ops.pack(
            jnp.broadcast_to(slot_ids.astype(jnp.uint32)[:, None], (T, WS)),
            jnp.broadcast_to(cts[:, None], (T, WS)))

        # ---- 5. stage the write-set CAS requests -------------------------
        wref = jnp.clip(batch.write_ref, 0, RS - 1)
        wslots = jnp.take_along_axis(read_slots, wref, axis=1)
        expected = jnp.take_along_axis(read_hdr, wref[:, :, None], axis=1)
        req_slots_g = wslots.reshape(-1)
        wloc, winside = _local_slots(req_slots_g, base, shard_records)
        req_active = (batch.write_mask
                      & (txn_found & active)[:, None]).reshape(-1)
        mine = req_active & winside
        prio = jnp.broadcast_to(
            batch.tid.astype(jnp.uint32)[:, None], (T, WS)).reshape(-1)
        txn_of_req = jnp.broadcast_to(
            jnp.arange(T, dtype=jnp.int32)[:, None], (T, WS)).reshape(-1)

        # ---- 6b. append the WAL intent records (§6.2 — before install) ---
        # every memory server writes the identical entry into its resident
        # replica: the "journal to more than one server" broadcast. Slots
        # are logged GLOBAL so any survivor can replay the whole pool. The
        # intent depends only on commit-phase INPUTS (never a CAS outcome),
        # so staging it before either commit rendering below leaves the
        # journal bytes identical on the fused and the unfused path.
        if with_journal:
            journal = wal.append_intent(
                journal, batch.tid, vec,
                *wal.pad_writes(journal, wslots, new_hdr,
                                new_data, req_active.reshape(T, WS)),
                round_no=jround, seq=jseq)

        with jax.named_scope("si.commit"):
            std_vis = type(oracle).make_visible is VectorOracle.make_visible
            if fused_commit:
                # ---- 5.-9. fused: the decide/apply double-launch (§8) -------
                # the same pure kernel runs twice per shard: a decide pass with
                # ext_fails = 0 whose only used output is this shard's
                # per-transaction failure counts (the psum is the global AND of
                # phase 6), then the apply pass replays the identical
                # tournament with ext_fails = total - local and writes the net
                # state transition — bit-equal to the unfused arbitrate → psum
                # → install → release rendering in the else-branch.
                from repro.kernels.commit import ops as commit_ops
                lslots = jnp.where(winside, wloc, 0)
                dec = commit_ops.fused_commit(
                    table, vec, lslots, expected.reshape(-1, 2), prio, mine,
                    txn_of_req, new_hdr.reshape(-1, 2),
                    new_data.reshape(-1, W),
                    txn_found & active, slot_ids, cts,
                    jnp.zeros((T,), jnp.int32))
                ext_fails = jax.lax.psum(dec.fails, axis) - dec.fails
                fc = commit_ops.fused_commit(
                    table, vec, lslots, expected.reshape(-1, 2), prio, mine,
                    txn_of_req, new_hdr.reshape(-1, 2),
                    new_data.reshape(-1, W),
                    txn_found & active, slot_ids, cts, ext_fails)
                table = fc.table
                granted = anno.tag(fc.granted, anno.LOCK_GRANTED)
                committed = anno.tag(fc.committed, anno.COMMIT_COMMITTED)
                do_install = fc.do_install
                release_mask = anno.tag(granted & ~committed[txn_of_req],
                                        anno.LOCK_RELEASED)
            else:
                # ---- 5. validate+lock on the owning shard -------------------
                res = cas.arbitrate(table.cur_hdr, jnp.where(winside, wloc, 0),
                                    expected.reshape(-1, 2), prio, mine)
                granted = anno.tag(res.granted, anno.LOCK_GRANTED)
                table = table._replace(cur_hdr=res.new_hdr)

                K = table.n_old
                vpos = jnp.mod(table.next_write[jnp.where(mine, wloc, 0)], K)
                victim = table.old_hdr[jnp.where(mine, wloc, 0), vpos]
                effective = granted & hdr_ops.is_moved(victim)

                # ---- 6. global commit decision (psum of failures) -----------
                failed_local = mine & ~effective
                fails = jnp.zeros((T,), jnp.int32).at[txn_of_req].add(
                    failed_local.astype(jnp.int32))
                fails = jax.lax.psum(fails, axis)
                committed = anno.tag((fails == 0) & txn_found & active,
                                     anno.COMMIT_COMMITTED)

                # ---- 7./8. install / release on the owning shard ------------
                do_install = effective & committed[txn_of_req]
                inst = mvcc.install(table, wloc, new_hdr.reshape(-1, 2),
                                    new_data.reshape(-1, W), do_install)
                table = inst.table
                release_mask = anno.tag(granted & ~committed[txn_of_req],
                                        anno.LOCK_RELEASED)
                table = table._replace(
                    cur_hdr=cas.release(table.cur_hdr, wloc, release_mask))
            n_installs = jax.lax.psum(jnp.sum(do_install.astype(jnp.int32)),
                                      axis)
            n_releases = jax.lax.psum(jnp.sum(release_mask.astype(jnp.int32)),
                                      axis)

        # ---- 9. make visible (identical update as the reference path) ----
        if with_journal:   # outcome record after the global decision (§3.2)
            journal = wal.append_outcome(journal, batch.tid, committed)
        with jax.named_scope("si.commit"):
            if fused_commit and std_vis:
                vec = fc.vec   # the kernel's in-launch scatter-max (phase 9)
            else:
                vec = oracle.make_visible(
                    VectorState(vec=vec), batch.tid, cts, committed).vec
            if shard_vector:
                if padded_slots != oracle.n_slots:
                    vec = jnp.concatenate(
                        [vec, jnp.zeros((padded_slots - oracle.n_slots,),
                                        vec.dtype)])
                vec = jax.lax.dynamic_slice_in_dim(
                    vec, shard_id * part_slots, part_slots)

        out = DistRoundOut(
            committed=committed, snapshot_miss=~txn_found,
            read_data=read_data, txn_found=txn_found,
            from_current=from_current, from_ovf=from_ovf,
            read_found=read_found, n_installs=n_installs,
            n_releases=n_releases)
        if with_journal:
            return table, vec, out, journal
        return table, vec, out

    tbl_spec = VersionedTable(
        cur_hdr=P(axis), cur_data=P(axis), old_hdr=P(axis), old_data=P(axis),
        next_write=P(axis), ovf_hdr=P(axis), ovf_data=P(axis),
        ovf_next=P(axis))
    batch_spec = TxnBatch(tid=P(), read_slots=P(), read_mask=P(),
                          write_ref=P(), write_mask=P())
    vec_spec = P(axis) if shard_vector else P()
    out_spec = DistRoundOut(
        committed=P(), snapshot_miss=P(), read_data=P(), txn_found=P(),
        from_current=P(), from_ovf=P(), read_found=P(), n_installs=P(),
        n_releases=P())
    # one journal replica resident per memory server; the append cursor is
    # maintained identically on every server (replicated)
    jnl_spec = wal.Journal(
        ts_vec=P(axis), slots=P(axis), new_hdr=P(axis), new_data=P(axis),
        write_mask=P(axis), committed=P(axis), resolved=P(axis),
        round_no=P(axis), seq=P(axis), used=P())
    jnl_specs = (jnl_spec, P(), P()) if with_journal else ()
    dir_specs = (P(axis), P(axis), P(), P()) if n_dir_buckets else ()
    out_specs = (tbl_spec, vec_spec, out_spec) \
        + ((jnl_spec,) if with_journal else ())
    fn = jax.jit(shard_map(local_round, mesh=mesh,
                           in_specs=(tbl_spec, vec_spec, batch_spec, P(), P())
                           + jnl_specs + dir_specs,
                           out_specs=out_specs, check_vma=False))

    def round_fn(table, vec, batch, aux, active=None, *, journal=None,
                 round_no=0, seq=0, directory=None, read_keys=None,
                 key_mask=None):
        if active is None:
            active = jnp.ones((batch.tid.shape[0],), bool)
        if (journal is not None) != with_journal:
            raise ValueError(
                "journal argument does not match the executor: build "
                f"distributed_round(with_journal={with_journal}) and pass "
                "a journal iff it is True")
        jargs = (journal, jnp.asarray(round_no, jnp.int32),
                 jnp.asarray(seq, jnp.int32)) if with_journal else ()
        if n_dir_buckets:
            return fn(table, vec, batch, aux, active, *jargs, directory.keys,
                      directory.vals, read_keys, key_mask)
        return fn(table, vec, batch, aux, active, *jargs)

    return round_fn, n_shards


class ReadOnlyOut(NamedTuple):
    """Replicated outputs of :func:`distributed_readonly_round`."""
    read_data: jnp.ndarray      # int32 [T, RS, W]
    found: jnp.ndarray          # bool  [T, RS] (True where masked out)
    from_current: jnp.ndarray   # bool  [T, RS]


def distributed_readonly_round(mesh: Mesh, axis: str, shard_records: int, *,
                               shard_vector: bool = False,
                               n_dir_buckets: int = 0,
                               dir_max_probes: int = 16):
    """Build a jittable snapshot-read executor over the sharded pool.

    Read-only transactions never validate under SI (paper §1.2): their whole
    execution is phase 1-2 of Listing 1 — fetch the timestamp vector, issue
    one-sided visible reads. This builder renders exactly that against the
    range-partitioned pool: masked local gathers on the owning memory server
    combined with an all-reduce, no CAS, no install, no visibility write; the
    table and vector pass through untouched.

    ``n_dir_buckets > 0`` adds the §5.2 key-addressed path (same contract as
    :func:`distributed_round`): ``ro_fn`` grows keyword arguments
    ``directory``/``read_keys``/``key_mask``, marked reads resolve their
    slots by probing the partitioned bucket array, and a directory miss
    reports not-found.

    Returns ``ro_fn(table, vec, read_slots, read_mask) -> ReadOnlyOut`` with
    ``read_slots`` int32 [T, RS] and ``read_mask`` bool [T, RS] replicated.
    """
    n_shards = mesh.shape[axis]
    if n_dir_buckets and n_dir_buckets % n_shards:
        raise ValueError(f"n_dir_buckets ({n_dir_buckets}) must divide over "
                         f"the mesh axis ({n_shards})")

    @jax.named_scope("si.read")
    def local_read(table: VersionedTable, vec: jnp.ndarray, read_slots,
                   read_mask, *dir_args):
        shard_id = jax.lax.axis_index(axis)
        base = shard_id * shard_records
        T, RS = read_slots.shape
        W = table.payload_width
        if shard_vector:
            vec = jax.lax.all_gather(vec, axis, tiled=True)
        if n_dir_buckets:
            dir_keys, dir_vals, read_keys, key_mask = dir_args
            dir_base = shard_id * (n_dir_buckets // n_shards)
            vsum, khit = ht.lookup_shard(
                dir_keys, dir_vals, read_keys.reshape(-1), dir_base,
                n_dir_buckets, max_probes=dir_max_probes)
            vsum = jax.lax.psum(vsum, axis)
            khit = jax.lax.psum(khit.astype(jnp.int32), axis) > 0
            kfound = khit & (vsum >= 0)
            km = key_mask.reshape(-1)
            flat = jnp.where(km, jnp.where(kfound, vsum, 0),
                             read_slots.reshape(-1))
            key_ok = ~km | kfound
        else:
            flat = read_slots.reshape(-1)
            key_ok = jnp.ones(flat.shape, bool)
        loc, inside = _local_slots(flat, base, shard_records)
        vr = mvcc.read_visible(table, jnp.where(inside, loc, 0), vec)
        rd = jax.lax.psum(jnp.where(inside[:, None], vr.data, 0), axis)
        fnd = (jax.lax.psum(
            jnp.where(inside, vr.found, False).astype(jnp.int32), axis) > 0) \
            & key_ok
        fcur = (jax.lax.psum(
            jnp.where(inside, vr.from_current, False).astype(jnp.int32),
            axis) > 0) & key_ok
        return ReadOnlyOut(
            read_data=rd.reshape(T, RS, W),
            found=fnd.reshape(T, RS) | ~read_mask,
            from_current=fcur.reshape(T, RS))

    tbl_spec = VersionedTable(
        cur_hdr=P(axis), cur_data=P(axis), old_hdr=P(axis), old_data=P(axis),
        next_write=P(axis), ovf_hdr=P(axis), ovf_data=P(axis),
        ovf_next=P(axis))
    vec_spec = P(axis) if shard_vector else P()
    out_spec = ReadOnlyOut(read_data=P(), found=P(), from_current=P())
    dir_specs = (P(axis), P(axis), P(), P()) if n_dir_buckets else ()
    fn = jax.jit(shard_map(local_read, mesh=mesh,
                           in_specs=(tbl_spec, vec_spec, P(), P())
                           + dir_specs,
                           out_specs=out_spec, check_vma=False))
    if not n_dir_buckets:
        return fn

    def ro_fn(table, vec, read_slots, read_mask, *, directory=None,
              read_keys=None, key_mask=None):
        if read_keys is None:       # slot-addressed call on a key engine
            read_keys = jnp.zeros(read_slots.shape, jnp.uint32)
            key_mask = jnp.zeros(read_slots.shape, bool)
        return fn(table, vec, read_slots, read_mask, directory.keys,
                  directory.vals, read_keys, key_mask)

    return ro_fn


# ---------------------------------------------------------------------------
# Distributed garbage collection: the per-memory-server §5.3 GC thread
# ---------------------------------------------------------------------------
def init_shard_logs(n_shards: int, n_snapshots: int,
                    n_slots: int) -> gc_ops.SnapshotLog:
    """Per-shard snapshot logs: one §5.3 :class:`~repro.core.gc.SnapshotLog`
    per memory server, stacked on a leading shard axis (sharded over the mesh
    by :func:`distributed_gc_round`'s in-specs)."""
    return gc_ops.SnapshotLog(
        times=jnp.full((n_shards, n_snapshots), -1, jnp.int32),
        vecs=jnp.zeros((n_shards, n_snapshots, n_slots), jnp.uint32))


def distributed_gc_round(mesh: Mesh, axis: str, *,
                         shard_vector: bool = False,
                         n_vec_slots: int | None = None):
    """Build a jittable per-shard GC sweep over the sharded pool (§5.3).

    Each memory-server shard runs :func:`repro.core.gc.gc_round` — snapshot
    the timestamp vector into its OWN :class:`~repro.core.gc.SnapshotLog`,
    derive the safe vector, sweep + lazily truncate — against only its
    resident records. With ``shard_vector=True`` the (range-partitioned)
    vector is first all-gathered, exactly as the round executor's snapshot
    read: every shard therefore logs the same full vector, so per-shard safe
    vectors coincide with the single-shard one and the sweep of shard-local
    rows is bit-identical to the single-shard sweep of the whole pool — GC
    preserves the placement-not-semantics equivalence contract
    (tests/test_distributed_equiv.py runs it inside the drivers' GC rounds).

    Returns ``gc_fn(table, vec, logs, now, max_txn_time) -> (table, logs)``
    with ``logs`` from :func:`init_shard_logs` (leading shard axis); ``now``
    and ``max_txn_time`` are traced scalars, so one compile serves the run.

    ``n_vec_slots`` is the oracle's true vector width: when the partitioned
    vector carries :func:`pad_vector` zeros (shard count does not divide the
    slot count), the gathered vector is sliced back to ``n_vec_slots`` so the
    snapshot log rows keep the exact oracle width.
    """

    def local_gc(table: VersionedTable, vec, log_times, log_vecs, now,
                 max_txn_time):
        if shard_vector:
            vec = jax.lax.all_gather(vec, axis, tiled=True)
            # drop the pad_vector zeros so the snapshot log entry has the
            # exact oracle width (non-dividing shard counts)
            if n_vec_slots is not None:
                vec = vec[:n_vec_slots]
        log = gc_ops.SnapshotLog(times=log_times[0], vecs=log_vecs[0])
        table, log = gc_ops.gc_round(table, vec, log, now, max_txn_time)
        return table, log.times[None], log.vecs[None]

    tbl_spec = VersionedTable(
        cur_hdr=P(axis), cur_data=P(axis), old_hdr=P(axis), old_data=P(axis),
        next_write=P(axis), ovf_hdr=P(axis), ovf_data=P(axis),
        ovf_next=P(axis))
    vec_spec = P(axis) if shard_vector else P()
    fn = jax.jit(shard_map(
        local_gc, mesh=mesh,
        in_specs=(tbl_spec, vec_spec, P(axis), P(axis), P(), P()),
        out_specs=(tbl_spec, P(axis), P(axis)), check_vma=False))

    def gc_fn(table, vec, logs: gc_ops.SnapshotLog, now, max_txn_time):
        table, times, vecs = fn(table, vec, logs.times, logs.vecs,
                                jnp.asarray(now, jnp.int32),
                                jnp.asarray(max_txn_time, jnp.int32))
        return table, gc_ops.SnapshotLog(times=times, vecs=vecs)

    return gc_fn


def pad_table(table: VersionedTable, multiple: int):
    """Pad the record axis so it divides evenly over ``multiple`` shards.

    Padding records are marked deleted (reads report not-found) and their
    old-version slots carry the reusable "moved" sentinel, same as
    :func:`repro.core.mvcc.init_table`; no transaction ever addresses them,
    they only square off the shard_map partitioning. Returns
    ``(padded_table, n_padded_records)``.
    """
    n = table.n_records
    pad = (-n) % multiple
    if pad == 0:
        return table, n
    filler = mvcc.init_table(pad, table.payload_width, n_old=table.n_old,
                             n_overflow=table.ovf_hdr.shape[1])
    filler = filler._replace(
        cur_hdr=hdr_ops.with_deleted(filler.cur_hdr, True))
    padded = jax.tree.map(lambda a, b: jnp.concatenate([a, b], axis=0),
                          table, filler)
    return padded, n + pad


def shard_table(mesh: Mesh, axis: str, table: VersionedTable):
    """Place a replicated-host table with its record axis sharded."""
    def put(x):
        return jax.device_put(
            x, NamedSharding(mesh, P(*([axis] + [None] * (x.ndim - 1)))))
    return jax.tree.map(put, table)


def pad_vector(vec: jnp.ndarray, multiple: int):
    """Zero-pad the timestamp vector so it divides evenly over ``multiple``
    memory servers — the vector analogue of :func:`pad_table` (a 3→5-style
    expansion need not divide the slot count). Pad slots are never addressed
    by any thread and are stripped after every all-gather, so they carry no
    semantics. Returns ``(padded_vec, n_padded_slots)``; the dividing case
    returns the input unchanged."""
    n = vec.shape[0]
    pad = (-n) % multiple
    if pad == 0:
        return vec, n
    return jnp.concatenate([vec, jnp.zeros((pad,), vec.dtype)]), n + pad


def shard_vector(mesh: Mesh, axis: str, vec: jnp.ndarray) -> jnp.ndarray:
    """Place the timestamp vector range-partitioned over the mesh axis
    (§4.2 "Partitioning of T_R" — pair with ``shard_vector=True``). The
    vector is :func:`pad_vector`-padded first so any shard count works."""
    vec, _ = pad_vector(vec, mesh.shape[axis])
    return jax.device_put(vec, NamedSharding(mesh, P(axis)))


def shard_journal(mesh: Mesh, axis: str, journal: wal.Journal) -> wal.Journal:
    """Place a §6.2 journal with its replica axis mapped over the mesh axis:
    one journal replica resident on each memory server, so a server failure
    leaves ``n_shards - 1`` identical survivors. ``n_replicas`` must equal
    the mesh-axis size; the append cursor stays replicated."""
    n_shards = mesh.shape[axis]
    if journal.n_replicas != n_shards:
        raise ValueError(
            f"journal has {journal.n_replicas} replicas but the {axis!r} "
            f"axis holds {n_shards} memory servers — init the journal with "
            f"n_replicas={n_shards}")

    def put(x):
        return jax.device_put(
            x, NamedSharding(mesh, P(*([axis] + [None] * (x.ndim - 1)))))

    entry_fields = ("ts_vec", "slots", "new_hdr", "new_data", "write_mask",
                    "committed", "resolved", "round_no", "seq")
    return journal._replace(
        used=jax.device_put(journal.used, NamedSharding(mesh, P())),
        **{f: put(getattr(journal, f)) for f in entry_fields})


# ---------------------------------------------------------------------------
# Online scale-out: re-place a live store onto a larger mesh (§6 elasticity)
# ---------------------------------------------------------------------------
def expand_mesh(mesh: Mesh, axis: str, table: VersionedTable,
                vec: jnp.ndarray, *, n_records: int,
                vector_sharded: bool = False,
                directory: ht.HashTable | None = None,
                journal: wal.Journal | None = None,
                gc_logs: gc_ops.SnapshotLog | None = None):
    """Re-place a live store's device state onto a (larger) mesh.

    This is the storage-layer half of online scale-out (DESIGN.md §4.3):
    given the merged post-migration record pool and timestamp vector as
    host/replicated arrays — ``table`` trimmed of any previous shard-count's
    :func:`pad_table` filler via ``n_records``, ``vec`` unpadded — it
    re-partitions every placed structure over the new mesh:

    - records: :func:`pad_table` to the new shard count, :func:`shard_table`;
    - timestamp vector (when ``vector_sharded``): :func:`shard_vector`
      (which re-pads for the new count);
    - §5.2 directory: :func:`shard_directory` over the new bucket ranges;
    - §6.2 journal: :func:`wal.grow_replicas` to one replica per new server
      (the broadcast journal is identical across replicas, so the joiners'
      replicas are exact copies), then :func:`shard_journal`;
    - §5.3 snapshot logs: every shard logs the identical full vector (see
      :func:`distributed_gc_round`), so the joiners' logs are copies of
      shard 0's.

    Returns ``(table, vec, directory, journal, gc_logs)`` with the optional
    structures passed through as ``None`` when not supplied.
    """
    n_shards = mesh.shape[axis]
    tbl = jax.tree.map(lambda x: x[:n_records], table)
    tbl, _ = pad_table(tbl, n_shards)
    tbl = shard_table(mesh, axis, tbl)
    if vector_sharded:
        vec = shard_vector(mesh, axis, vec)
    if directory is not None:
        directory = shard_directory(mesh, axis, directory)
    if journal is not None:
        journal = shard_journal(mesh, axis,
                                wal.grow_replicas(journal, n_shards))
    if gc_logs is not None:
        gc_logs = gc_ops.SnapshotLog(
            times=jnp.repeat(jnp.asarray(gc_logs.times)[:1], n_shards, 0),
            vecs=jnp.repeat(jnp.asarray(gc_logs.vecs)[:1], n_shards, 0))
    return tbl, vec, directory, journal, gc_logs
