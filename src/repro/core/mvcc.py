"""Multi-version record storage (paper §5.1, Figure 3).

Layout per table (R record slots, payload width W int32 words, K old-version
slots, KO overflow slots):

* ``cur_hdr  uint32 [R, 2]``, ``cur_data int32 [R, W]`` — the *current
  version*, stored in place so the common case is ONE one-sided read; a
  contiguous region so scans are one bulk read.
* ``old_hdr  uint32 [R, K, 2]``, ``old_data int32 [R, K, W]`` — the circular
  *old-version buffers*, header and data split (paper: headers are fetched
  alone first to locate a version, then exactly one payload read follows).
* ``next_write int32 [R]`` — the circular buffers' next-write counter.
* ``ovf_hdr/ovf_data [R, KO, …]``, ``ovf_next int32 [R]`` — the overflow
  region fed by the version-mover thread. ``ovf_next`` is the ring's
  next-write *position* (always in ``[0, KO)`` — bounded by construction);
  under the §5.3 GC discipline (``version_mover(reuse_only=True)``) the
  mover only ever advances into slots whose deleted bit is set, i.e. slots
  reclaimed by :func:`repro.core.gc.collect` and lazily truncated by
  :func:`compact_overflow`.

Fixed-length payloads only, exactly as the paper's current implementation
(§5.1 "Record Layout"); our TPC-C encodes every column into int32 words.

The header/payload split is also the kernel contract (DESIGN.md §8): the
Pallas kernels in ``repro.kernels.{hash_probe,commit}`` stage the
``[·, 2]`` header planes in exactly this interleaved layout (the old ring
flattened row-major) and never see a payload — ``locate_visible`` /
``gather_version`` define the locator the batched probe emits, and the
commit kernel's install scatter mirrors :func:`install`'s header path
with payloads applied outside the launch.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from repro.core import header as hdr_ops


class VersionedTable(NamedTuple):
    cur_hdr: jnp.ndarray    # uint32 [R, 2]
    cur_data: jnp.ndarray   # int32  [R, W]
    old_hdr: jnp.ndarray    # uint32 [R, K, 2]
    old_data: jnp.ndarray   # int32  [R, K, W]
    next_write: jnp.ndarray  # int32 [R]
    ovf_hdr: jnp.ndarray    # uint32 [R, KO, 2]
    ovf_data: jnp.ndarray   # int32  [R, KO, W]
    ovf_next: jnp.ndarray   # int32 [R]

    @property
    def n_records(self) -> int:
        return self.cur_hdr.shape[0]

    @property
    def payload_width(self) -> int:
        return self.cur_data.shape[1]

    @property
    def n_old(self) -> int:
        return self.old_hdr.shape[1]


def init_table(n_records: int, payload_width: int, n_old: int = 4,
               n_overflow: int = 8) -> VersionedTable:
    """Fresh table: version 0 by thread 0, all old slots moved (=reusable)."""
    cur_hdr = hdr_ops.pack(
        jnp.zeros((n_records,), jnp.uint32), jnp.zeros((n_records,), jnp.uint32)
    )
    old_hdr = hdr_ops.pack(
        jnp.zeros((n_records, n_old), jnp.uint32),
        jnp.zeros((n_records, n_old), jnp.uint32),
        moved=jnp.ones((n_records, n_old), bool),
    )
    ovf_hdr = hdr_ops.pack(
        jnp.zeros((n_records, n_overflow), jnp.uint32),
        jnp.zeros((n_records, n_overflow), jnp.uint32),
        deleted=jnp.ones((n_records, n_overflow), bool),
    )
    return VersionedTable(
        cur_hdr=cur_hdr,
        cur_data=jnp.zeros((n_records, payload_width), jnp.int32),
        old_hdr=old_hdr,
        old_data=jnp.zeros((n_records, n_old, payload_width), jnp.int32),
        next_write=jnp.zeros((n_records,), jnp.int32),
        ovf_hdr=ovf_hdr,
        ovf_data=jnp.zeros((n_records, n_overflow, payload_width), jnp.int32),
        ovf_next=jnp.zeros((n_records,), jnp.int32),
    )


def read_current(tbl: VersionedTable, slots):
    """The common-case single one-sided read: header + payload in place."""
    return tbl.cur_hdr[slots], tbl.cur_data[slots]


class VisibleRead(NamedTuple):
    hdr: jnp.ndarray     # uint32 [Q, 2] — header of the chosen version
    data: jnp.ndarray    # int32  [Q, W]
    found: jnp.ndarray   # bool [Q] — False ⇒ snapshot too old (GC'd) → abort
    from_current: jnp.ndarray  # bool [Q] — stats: hit the in-place version
    from_ovf: jnp.ndarray      # bool [Q] — stats: served by the overflow
    #                            region (a GC-survivor old version)


# locate_visible source codes: which region serves the chosen version
SRC_CURRENT = 0
SRC_OLD = 1
SRC_OVF = 2


class VersionLoc(NamedTuple):
    """Locator of the newest version visible under T_R — region + position.

    The definitional §5.1 resolution order (current → old ring → overflow),
    shared by :func:`read_visible` (which gathers header/payload through it)
    and by the fused hash-probe kernel's oracle
    (:func:`repro.kernels.hash_probe.ref.hash_probe_ref`), so the two can
    never diverge. When ``found`` is False the locator still points at a
    deterministic position (the newest overflow slot) — callers must gate on
    ``found`` before trusting the payload, exactly like a GC'd snapshot read.
    """
    found: jnp.ndarray   # bool [Q]
    src: jnp.ndarray     # int32 [Q] — SRC_CURRENT / SRC_OLD / SRC_OVF
    pos: jnp.ndarray     # int32 [Q] — ring position (0 for SRC_CURRENT)


def _ring_scan(region_hdr, next_ptr, slots, ts_vec, *, skip_sentinel: bool):
    """Newest-first visibility scan of one circular version region — THE
    selection rule of §5.1, shared by :func:`locate_visible` and
    :func:`read_visible` so the fused kernel's oracle and the unfused
    engine path cannot diverge. A version is usable iff visible(⟨i,t⟩, T_R)
    and not deleted; with ``skip_sentinel`` (the old-version ring) a
    never-written slot's zero/moved sentinel header — cts 0, thread 0,
    moved=1 — is excluded even though cts 0 is always visible.

    Returns ``(pos [Q,K], hdr [Q,K,2], ok [Q,K], first [Q], any [Q])``:
    circular positions newest→oldest, the scanned headers, the usable mask,
    argmax(ok) (= the newest usable version's age) and its validity.
    """
    K = region_hdr.shape[1]
    nx = next_ptr[slots]                             # [Q]
    ages = jnp.arange(K, dtype=jnp.int32)            # 0 = newest
    pos = jnp.mod(nx[:, None] - 1 - ages[None, :], K)  # [Q, K]
    h = region_hdr[slots[:, None], pos]              # [Q, K, 2]
    ok = hdr_ops.visible(h, ts_vec) & ~hdr_ops.is_deleted(h)
    if skip_sentinel:
        is_sentinel = (hdr_ops.commit_ts(h) == 0) \
            & (hdr_ops.thread_id(h) == 0) & hdr_ops.is_moved(h)
        ok = ok & ~is_sentinel
    # analysis: safe(W03): boolean visibility-mask operand — no sentinels
    return pos, h, ok, jnp.argmax(ok, axis=1), jnp.any(ok, axis=1)


def locate_visible(tbl: VersionedTable, slots, ts_vec) -> VersionLoc:
    """Headers-only §5.1 resolution: (1) current version; (2) old-version
    ring, newest→oldest by circular position; (3) overflow ring."""
    slots = jnp.asarray(slots, jnp.int32)
    cur_h = tbl.cur_hdr[slots]
    cur_ok = hdr_ops.visible(cur_h, ts_vec) & ~hdr_ops.is_deleted(cur_h)
    pos, _, _, first, any_old = _ring_scan(
        tbl.old_hdr, tbl.next_write, slots, ts_vec, skip_sentinel=True)
    old_pos = jnp.take_along_axis(pos, first[:, None], axis=1)[:, 0]
    opos, _, _, vfirst, any_ovf = _ring_scan(
        tbl.ovf_hdr, tbl.ovf_next, slots, ts_vec, skip_sentinel=False)
    ovf_pos = jnp.take_along_axis(opos, vfirst[:, None], axis=1)[:, 0]

    src = jnp.where(cur_ok, SRC_CURRENT,
                    jnp.where(any_old, SRC_OLD, SRC_OVF)).astype(jnp.int32)
    loc_pos = jnp.where(cur_ok, 0, jnp.where(any_old, old_pos, ovf_pos))
    return VersionLoc(found=cur_ok | any_old | any_ovf, src=src,
                      pos=loc_pos.astype(jnp.int32))


def gather_version(tbl: VersionedTable, slots, loc: VersionLoc):
    """Fetch (hdr, data) of the version a :class:`VersionLoc` points at —
    the paper's 'exactly one payload read follows' step: one gather per
    region instead of materializing every ring version."""
    slots = jnp.asarray(slots, jnp.int32)
    cur_h, cur_d = read_current(tbl, slots)
    old_h = tbl.old_hdr[slots, loc.pos]
    old_d = tbl.old_data[slots, loc.pos]
    ovf_h = tbl.ovf_hdr[slots, loc.pos]
    ovf_d = tbl.ovf_data[slots, loc.pos]
    is_cur = (loc.src == SRC_CURRENT)[:, None]
    is_old = (loc.src == SRC_OLD)[:, None]
    hdr = jnp.where(is_cur, cur_h, jnp.where(is_old, old_h, ovf_h))
    data = jnp.where(is_cur, cur_d, jnp.where(is_old, old_d, ovf_d))
    return hdr, data


def read_visible(tbl: VersionedTable, slots, ts_vec) -> VisibleRead:
    """Find the newest version visible under T_R (paper §4.1 + §5.1).

    Order of attempts mirrors the RDMA access pattern: (1) current version —
    one read; (2) old-version buffer headers, newest→oldest by circular
    position; (3) overflow region. A version is usable if visible(⟨i,t⟩, T_R)
    and not deleted.

    This is the *unfused* rendering: every ring version's header AND payload
    is materialized before the selection — the batched-vectorized analogue
    of reading whole version buffers. The fused hash-probe kernel
    (``repro.kernels.hash_probe``) implements the same resolution via
    :func:`locate_visible` + :func:`gather_version` — headers alone first,
    then exactly one payload read (§5.1's stated discipline) — and
    ``bench_kernels.py`` measures the gap. The two selections share the
    visibility logic through :func:`locate_visible`'s contract and are
    proven bit-identical in tests/test_kernels.py.
    """
    slots = jnp.asarray(slots, jnp.int32)
    cur_h, cur_d = read_current(tbl, slots)
    cur_ok = hdr_ops.visible(cur_h, ts_vec) & ~hdr_ops.is_deleted(cur_h)

    # ---- old-version circular buffer, scanned newest first -------------
    pos, oh, ok, first, any_old = _ring_scan(
        tbl.old_hdr, tbl.next_write, slots, ts_vec, skip_sentinel=True)
    od = tbl.old_data[slots[:, None], pos]           # [Q, K, W]
    old_h = jnp.take_along_axis(oh, first[:, None, None], axis=1)[:, 0]
    old_d = jnp.take_along_axis(od, first[:, None, None], axis=1)[:, 0]

    # ---- overflow region (oldest versions) ------------------------------
    opos, vh, vok, vfirst, any_ovf = _ring_scan(
        tbl.ovf_hdr, tbl.ovf_next, slots, ts_vec, skip_sentinel=False)
    vd = tbl.ovf_data[slots[:, None], opos]
    ovf_h = jnp.take_along_axis(vh, vfirst[:, None, None], axis=1)[:, 0]
    ovf_d = jnp.take_along_axis(vd, vfirst[:, None, None], axis=1)[:, 0]

    hdr = jnp.where(cur_ok[:, None], cur_h,
                    jnp.where(any_old[:, None], old_h, ovf_h))
    data = jnp.where(cur_ok[:, None], cur_d,
                     jnp.where(any_old[:, None], old_d, ovf_d))
    found = cur_ok | any_old | any_ovf
    return VisibleRead(hdr=hdr, data=data, found=found, from_current=cur_ok,
                       from_ovf=~cur_ok & ~any_old & any_ovf)


class InstallResult(NamedTuple):
    table: VersionedTable
    installed: jnp.ndarray  # bool [Q] — False ⇒ old-slot not reusable yet


def install(tbl: VersionedTable, slots, new_hdr, new_data, mask) -> InstallResult:
    """Install write-set versions in place (paper §5.1 "Version Management").

    Callers hold the lock on every masked slot (granted by cas.arbitrate), so
    masked slots are pairwise distinct and scatters are conflict-free. Steps,
    per record: (1) check the circular slot at ``next_write`` has moved=1 —
    else the install must wait (we abort-and-retry, returning installed=False
    after releasing the lock upstream); (2) copy the current version into the
    circular buffers; (3) write the new current version with the lock bit
    cleared; (4) bump next_write.
    """
    slots = jnp.asarray(slots, jnp.int32)
    safe = jnp.where(mask, slots, 0)
    K = tbl.n_old
    nw = tbl.next_write[safe]
    wpos = jnp.mod(nw, K)
    victim = tbl.old_hdr[safe, wpos]                  # slot to overwrite
    reusable = hdr_ops.is_moved(victim)
    do = mask & reusable

    # Masked-out requests are routed OUT OF BOUNDS and dropped by the scatter
    # (mode='drop'), so they can never alias a real record's update. Active
    # requests hold locks (cas.arbitrate grants exclusively), hence are
    # pairwise-distinct and the scatters below are conflict-free.
    idx = jnp.where(do, safe, tbl.n_records)
    cur_h = tbl.cur_hdr[safe]
    cur_d = tbl.cur_data[safe]
    # (2) move current → old buffer (moved=0: not yet copied to overflow)
    moved_h = hdr_ops.with_moved(hdr_ops.with_lock(cur_h, False), False)
    old_hdr = tbl.old_hdr.at[idx, wpos].set(moved_h, mode="drop")
    old_data = tbl.old_data.at[idx, wpos].set(cur_d, mode="drop")
    # (3) new current version, lock cleared in the same 8-byte write
    inst_h = hdr_ops.with_lock(new_hdr, False)
    cur_hdr2 = tbl.cur_hdr.at[idx].set(inst_h, mode="drop")
    cur_data2 = tbl.cur_data.at[idx].set(new_data, mode="drop")
    # (4) bump the circular counter
    next_write = tbl.next_write.at[idx].add(1, mode="drop")
    return InstallResult(
        table=tbl._replace(cur_hdr=cur_hdr2, cur_data=cur_data2,
                           old_hdr=old_hdr, old_data=old_data,
                           next_write=next_write),
        installed=do,
    )


def version_mover(tbl: VersionedTable, budget_per_record: int = 1, *,
                  reuse_only: bool = False) -> VersionedTable:
    """The memory-server version-mover thread (paper §5.1 + §5.3).

    Copies the OLDEST not-yet-moved old-buffer version of every record into
    the overflow region and sets its moved bit, freeing the slot for reuse.
    Runs continuously on memory servers; here one sweep per call.

    The overflow region is a ring: insertion advances strictly one slot at a
    time, so circular position order IS version age order (read_visible's
    newest-first scan depends on this). ``reuse_only`` selects the §5.3
    sustained-execution discipline: the mover advances only into slots whose
    deleted bit is set — i.e. slots reclaimed by the GC sweep
    (:func:`repro.core.gc.collect`) — and otherwise *stalls*, which
    backpressures :func:`install` into abort-and-retry instead of silently
    overwriting a version some admissible snapshot may still need. With
    ``reuse_only=False`` (the pre-GC behaviour, fine for short runs) the ring
    head is overwritten unconditionally, losing the oldest overflow version
    on wrap.
    """
    for _ in range(budget_per_record):
        K = tbl.n_old
        KO = tbl.ovf_hdr.shape[1]
        # oldest occupied position = next_write (mod K) scanning forward for
        # the first not-moved slot
        ages = jnp.arange(K, dtype=jnp.int32)
        pos = jnp.mod(tbl.next_write[:, None] + ages[None, :], K)  # old→new
        h = _pick_slot(tbl.old_hdr, pos)
        not_moved = ~hdr_ops.is_moved(h)
        # analysis: safe(W03): boolean not-moved mask operand — no sentinels
        first = jnp.argmax(not_moved, axis=1)
        has = jnp.any(not_moved, axis=1)
        src = _pick_slot(pos, first)
        mh = _pick_slot(tbl.old_hdr, src)
        md = _pick_slot(tbl.old_data, src)
        # append to overflow ring (reclaimed-slot allocation under GC)
        opos = jnp.mod(tbl.ovf_next, KO)
        if reuse_only:
            has = has & hdr_ops.is_deleted(_pick_slot(tbl.ovf_hdr, opos))
        to_ovf = (jnp.arange(KO)[None, :] == opos[:, None]) & has[:, None]
        ovf_hdr = jnp.where(to_ovf[..., None],
                            hdr_ops.with_deleted(mh, False)[:, None, :],
                            tbl.ovf_hdr)
        ovf_data = jnp.where(to_ovf[..., None], md[:, None, :], tbl.ovf_data)
        ovf_next = jnp.mod(tbl.ovf_next + has.astype(jnp.int32), KO)
        # set moved bit in the old buffer (slot stays readable until reused)
        moved = (jnp.arange(K)[None, :] == src[:, None]) & has[:, None]
        old_hdr = jnp.where(moved[..., None],
                            hdr_ops.with_moved(mh, True)[:, None, :],
                            tbl.old_hdr)
        tbl = tbl._replace(old_hdr=old_hdr, ovf_hdr=ovf_hdr,
                           ovf_data=ovf_data, ovf_next=ovf_next)
    return tbl


def _pick_slot(a, idx):
    """``a[r, idx[r, ...]]`` for every record ``r``: ``a`` is ``[R, K, ...]``
    and ``idx`` is ``[R, ...]`` with values in ``[0, K)``.

    Each record reads only its own K slots, so this is a select over the K
    slots rather than a gather over the table: a table-wide gather or
    scatter with one index per record takes the TPU compiler minutes at
    some table sizes (about 520 s for the version mover over 624,364
    records), while selects compile at once and stream the table."""
    sel = idx.reshape(idx.shape + (1,) * (a.ndim - 2))
    out = None
    for k in range(a.shape[1]):
        v = a[:, k].reshape((a.shape[0],) + (1,) * (idx.ndim - 1)
                            + a.shape[2:])
        out = v if out is None else jnp.where(sel == k, v, out)
    return jnp.broadcast_to(out, idx.shape + a.shape[2:])


def compact_overflow(tbl: VersionedTable) -> VersionedTable:
    """Lazy truncation of GC-marked overflow versions (paper §5.3).

    The paper truncates deleted versions lazily once contiguous regions free
    up; in the bounded ring the equivalent compaction resets every
    deleted-bit slot to the reusable sentinel — zero header and payload with
    only the deleted bit kept — physically reclaiming the space the mover's
    ring allocation will hand out next. Idempotent and read-invisible
    (deleted versions are never returned by read_visible).
    """
    dead = hdr_ops.is_deleted(tbl.ovf_hdr)                    # [R, KO]
    sentinel = hdr_ops.pack(jnp.uint32(0), jnp.uint32(0), deleted=True)
    return tbl._replace(
        ovf_hdr=jnp.where(dead[..., None], sentinel, tbl.ovf_hdr),
        ovf_data=jnp.where(dead[..., None], 0, tbl.ovf_data))
